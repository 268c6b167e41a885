"""Serving entry points.

``--mode gw`` (default) launches the GW solve server
(:mod:`repro.serve`): a synthetic catalog-matching workload is driven
through :class:`~repro.serve.GWServer` — size-bucketed batching, the
content-hash geometry cache, per-request health status — and the
server's metrics summary is printed. This is the CLI face of the
serving layer (DESIGN.md §9); ``benchmarks/bench_serve.py`` is its
measurement-grade sibling.

``--mode lm`` keeps the original LM serving loop: batched prefill +
decode with a KV/state cache, plus a GW-distance scoring mode (the
paper's technique as a serving feature — structural similarity between
the hidden geometries of request batches). ``generate`` and
``gw_similarity`` remain importable from here (tests/test_system.py,
examples/serve_lm_demo.py).

Usage (CPU examples):
  PYTHONPATH=src python -m repro.launch.serve --requests 16 --max-batch 8
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch smollm-135m \
      --reduced --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cb
from repro.core.align import gw_alignment_loss
from repro.models.model_zoo import Model


# ---------------------------------------------------------------------------
# GW solve-server mode
# ---------------------------------------------------------------------------

def _demo_geometry(n: int, seed: int):
    import repro
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, 2)).astype(np.float32)
    C = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1)).astype(np.float32)
    return repro.Geometry(jnp.asarray(C),
                          jnp.full(n, 1.0 / n, jnp.float32))


def gw_main(args) -> None:
    """Drive a synthetic catalog workload through GWServer and print the
    per-request outcomes + the metrics summary."""
    import repro
    from repro.serve import GWServer, ServeConfig, enable_compilation_cache

    print(f"compilation cache: {enable_compilation_cache()}")
    http_server = None
    if getattr(args, "metrics_port", 0):
        from repro.obs import serve_metrics_http
        http_server = serve_metrics_http(args.metrics_port)
        host, port = http_server.server_address[:2]
        print(f"metrics: http://{host}:{port}/metrics "
              f"(Prometheus text format)")

    server = GWServer(ServeConfig(max_batch=args.max_batch,
                                  max_wait_s=args.max_wait,
                                  on_failure=args.on_failure))
    solver = repro.get_solver(args.solver).default_config(64)
    needs_key = getattr(type(solver), "requires_key", False)

    reference = _demo_geometry(32, seed=999)
    sizes = (12, 18, 24, 28)
    t0 = time.time()
    rids = []
    for i in range(args.requests):
        query = _demo_geometry(sizes[i % len(sizes)], seed=100 + i % 6)
        problem = repro.QuadraticProblem(query, reference)
        key = jax.random.PRNGKey(i) if needs_key else None
        rids.append(server.submit(problem, solver, key=key))
    results = server.results(rids)
    dt = time.time() - t0

    for r in results:
        print(f"  rid={r.rid:3d} shape={r.shape} -> bucket{r.padded_shape} "
              f"value={r.value:.5f} status={r.status_name}"
              f"{' (fallback)' if r.fell_back else ''} "
              f"latency={r.latency_s * 1e3:.1f}ms")
    print(f"served {len(results)} requests in {dt:.2f}s "
          f"({len(results) / dt:.1f} req/s)")
    stats = server.stats()
    for k in sorted(stats):
        v = stats[k]
        print(f"  {k} = {v:.4f}" if isinstance(v, float) else
              f"  {k} = {v}")


# ---------------------------------------------------------------------------
# LM serving mode (legacy entry, kept importable)
# ---------------------------------------------------------------------------

def generate(model: Model, params, prompts, max_new: int,
             act_dtype=jnp.float32, temperature: float = 0.0, img=None,
             rng=None):
    """prompts: (B, S0) int32. Greedy (or sampled) continuation.

    Decode runs against a cache of length S0 + max_new; prefill fills the
    first S0 entries (written into the padded cache functionally).
    """
    B, S0 = prompts.shape[0], prompts.shape[1]
    total = S0 + max_new
    cache = model.init_cache(B, total, dtype=act_dtype)

    decode = jax.jit(
        lambda p, tok, c, idx: model.decode_step(p, tok, c, idx, img=img,
                                                 act_dtype=act_dtype))

    # teacher-forced prefill via decode steps on the padded cache (exact);
    # a fused prefill kernel is the production path for long prompts.
    tok = prompts[:, :1] if prompts.ndim == 2 else prompts[:, :1, :]
    logits = None
    for t in range(S0):
        logits, cache = decode(params, prompts[:, t:t + 1], cache,
                               jnp.int32(t))
    out = [prompts]
    rng = rng or jax.random.PRNGKey(0)
    for t in range(S0, total):
        if temperature > 0:
            rng, k = jax.random.split(rng)
            nxt = jax.random.categorical(k, logits[:, -1] / temperature,
                                         axis=-1)[:, None]
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(nxt.astype(jnp.int32))
        logits, cache = decode(params, nxt.astype(jnp.int32), cache,
                               jnp.int32(t))
    return jnp.concatenate(out, axis=1)


def gw_similarity(model: Model, params, batch_a, batch_b, s: int = 32,
                  act_dtype=jnp.float32):
    """GW distance between the hidden geometries of two request batches."""
    _, h_a, _ = model.forward(params, batch_a, act_dtype=act_dtype)
    _, h_b, _ = model.forward(params, batch_b, act_dtype=act_dtype)
    return gw_alignment_loss(jax.random.PRNGKey(0), h_a, h_b, s_r=s, s_c=s)


def lm_main(args) -> None:
    cfg = cb.get_reduced(args.arch) if args.reduced else cb.get_arch(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(7)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    seqs = generate(model, params, prompts, args.gen)
    dt = time.time() - t0
    print(f"generated {seqs.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    if args.metric == "gw":
        sim = gw_similarity(model, params, prompts,
                            jnp.flip(prompts, axis=0))
        print(f"GW(batch, reversed-batch) = {float(sim):.5f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("gw", "lm"), default="gw",
                    help="gw: GW solve server demo (default); lm: batched "
                         "LM generation loop")
    gw = ap.add_argument_group("gw mode")
    gw.add_argument("--requests", type=int, default=16)
    gw.add_argument("--solver", default="dense_gw")
    gw.add_argument("--max-batch", type=int, default=8)
    gw.add_argument("--max-wait", type=float, default=0.02)
    gw.add_argument("--on-failure", choices=("none", "fallback"),
                    default="fallback")
    gw.add_argument("--metrics-port", type=int, default=0,
                    help="serve the process metrics registry as Prometheus "
                         "text on this port (0 = off)")
    lm = ap.add_argument_group("lm mode")
    lm.add_argument("--arch", default=None)
    lm.add_argument("--reduced", action="store_true")
    lm.add_argument("--batch", type=int, default=4)
    lm.add_argument("--prompt-len", type=int, default=32)
    lm.add_argument("--gen", type=int, default=16)
    lm.add_argument("--metric", choices=("none", "gw"), default="none")
    args = ap.parse_args()
    if args.mode == "lm":
        if args.arch is None:
            ap.error("--mode lm requires --arch")
        lm_main(args)
    else:
        gw_main(args)


if __name__ == "__main__":
    main()
