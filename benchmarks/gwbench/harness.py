"""The benchmark's machinery: cells, traffic, warm-up, the check.

Everything that belongs to one configuration, traffic mix, cell, metric
or kind of solver lives in a file of its own under this directory and is
found by the name that ``BENCHMARK.json`` or a data file gives it:

    configs/<config>.json         a deployment: geometry, pinned solver,
                                  optional ``problem`` terms (lam,
                                  fused_penalty)
    traffic/<traffic>.json        a mix: loop, requests in flight, pool,
                                  order, ServeConfig fields, check sample
    limits/<workload>.json        the limit of each number compared
    geometries/<generator>.py     a config's ``geometry.generator``
    pools/<kind>.py               a traffic's ``pool.kind``: the problems
    loops/<kind>.py               a traffic's ``loop``: how load is offered
    families/<family>.py          a config's ``solver.family``: served
                                  answer, plain reference, numbers compared;
                                  ``<family>.<variant>.py`` where the
                                  ``problem`` block makes the problem fused
                                  or unbalanced
    end_to_end/<metric>.py        a reader: ``read(ctx) -> float``
    layer_metrics/<metric>.py     a reader: ``read(ctx) -> float | None``

A name with no file is an error, never a default. This module imports
nothing of the program at import time; the program (``repro``, under
``src/`` of the checkout) is imported by the functions that drive it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

HEALTHY = ("CONVERGED", "MAXITER")

# a config's ``problem`` block: QuadraticProblem field -> the variant of
# the problem it makes, which names the family's reference file
PROBLEM_TERMS = {"fused_penalty": "fused", "lam": "unbalanced"}

# what a geometry plugin's side may hold (``pair``/``collection`` return a
# (relation, weights) tuple or a dict of these)
SIDE_KEYS = ("relation", "points", "weights", "features")


# ---------------------------------------------------------------------------
# cells and the files found by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    return json.loads(path.read_text())


_PLUGINS: Dict[tuple, Any] = {}


def plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of this directory, loaded once."""
    if (kind, name) in _PLUGINS:
        return _PLUGINS[kind, name]
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (HERE / kind).glob("*.py"))
        raise FileNotFoundError(f"no {kind} named {name!r} ({path}); "
                                f"known: {known}")
    mod_name = f"gwbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _PLUGINS[kind, name] = mod
    return mod


def load_layer_metric(name: str):
    """The reader module of one per-layer metric, by its name."""
    return plugin("layer_metrics", name)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def family(self):
        """The solver family's module: served answer, reference, numbers."""
        return plugin("families", family_name(self.config))

    @property
    def loop(self):
        """The traffic's loop module: how load is offered."""
        return plugin("loops", self.traffic["loop"])


def problem_terms(config: dict) -> dict:
    """The config's ``problem`` block: the QuadraticProblem fields that the
    deployment pins beyond the geometries and the loss. A key that is no
    such field is an error, never ignored."""
    terms = dict(config.get("problem", {}))
    unknown = sorted(set(terms) - set(PROBLEM_TERMS))
    if unknown:
        raise ValueError(f"unknown problem terms {unknown}; known: "
                         f"{sorted(PROBLEM_TERMS)}")
    if "lam" in terms and not terms["lam"] > 0:
        raise ValueError(f"lam must be > 0, got {terms['lam']!r}")
    if "fused_penalty" in terms and not 0 < terms["fused_penalty"] <= 1:
        raise ValueError(f"fused_penalty must lie in (0, 1], got "
                         f"{terms['fused_penalty']!r}")
    return terms


def family_name(config: dict) -> str:
    """The family file of a config: its ``solver.family``, followed by the
    variant of each problem term set (``spar_gw.unbalanced``), so that a
    balanced reference never checks a fused or unbalanced problem."""
    terms = problem_terms(config)
    return ".".join([config["solver"]["family"]]
                    + [v for k, v in PROBLEM_TERMS.items() if k in terms])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell named ``name`` with its configuration, traffic mix,
    limits, and the metrics it reports."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name)
             and m["moves"] in reported]
    return Cell(name=name, workload=w, config=_json(ROOT / cfg["file"]),
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                limits=_json(HERE / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer)


# ---------------------------------------------------------------------------
# traffic: what a seed turns into
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Traffic:
    """The requests a seed makes: geometries, the pairs of them that the
    pool holds and, for request i, the pair it solves and its PRNG seed."""
    geoms: List[tuple]            # (relation matrix or None, marginal)
    pairs: List[tuple]            # (x geometry, y geometry) indices
    order: np.ndarray             # pair of request i (cycled)
    key_seeds: np.ndarray         # PRNG seed of request i (cycled)
    sides: List[dict]             # each geometry whole: SIDE_KEYS
    problem_terms: dict           # the config's ``problem`` block

    def pair_of(self, i: int) -> tuple:
        return self.pairs[int(self.order[i % len(self.order)])]

    def problem_data(self, i: int) -> tuple:
        """(Cx, a, Cy, b) of request i."""
        ix, iy = self.pair_of(i)
        return self.geoms[ix] + self.geoms[iy]

    def geometry_data(self, i: int) -> tuple:
        """Both sides of request i whole: dicts of SIDE_KEYS."""
        ix, iy = self.pair_of(i)
        return self.sides[ix], self.sides[iy]

    def key_seed(self, i: int) -> int:
        return int(self.key_seeds[i % len(self.key_seeds)])


# the order in which requests visit the pool's pairs, cycled
ORDERS = {
    "cycle": lambda rng, n: np.arange(n),
    "shuffle": lambda rng, n: rng.permutation(n),
}


def as_side(geom) -> dict:
    """One side of a problem as a geometry plugin gives it, a (relation,
    weights) tuple or a dict of SIDE_KEYS, as a checked dict."""
    if isinstance(geom, tuple):
        relation, weights = geom
        return {"relation": relation, "weights": weights}
    unknown = sorted(set(geom) - set(SIDE_KEYS))
    if unknown:
        raise ValueError(f"unknown geometry keys {unknown}; known: "
                         f"{list(SIDE_KEYS)}")
    if "weights" not in geom or ("relation" not in geom
                                 and "points" not in geom):
        raise ValueError("a geometry needs weights and a relation or "
                         f"points; got {sorted(geom)}")
    return dict(geom)


def build_traffic(config: dict, traffic: dict, seed: int) -> Traffic:
    """Geometries and request order from ``seed`` alone: the config's
    geometry generator fills the traffic's pool, then the pairs are
    ordered and each request gets its PRNG seed."""
    if traffic["order"] not in ORDERS:
        raise ValueError(f"unknown order {traffic['order']!r}; known: "
                         f"{sorted(ORDERS)}")
    terms = problem_terms(config)
    rng = np.random.default_rng(seed)
    geometry = plugin("geometries", config["geometry"]["generator"])
    pool = plugin("pools", traffic["pool"]["kind"])
    geoms, pairs = pool.build(geometry, config["geometry"], traffic["pool"],
                              rng)
    sides = [as_side(g) for g in geoms]
    if {"features" in side for side in sides} - {"fused_penalty" in terms}:
        raise ValueError("node features on every side and fused_penalty "
                         "in the problem block go together")
    order = ORDERS[traffic["order"]](rng, len(pairs))
    key_seeds = rng.integers(0, 2**31 - 1, size=max(len(order), 4096))
    return Traffic(geoms=[(side.get("relation"), side["weights"])
                          for side in sides],
                   pairs=pairs, order=order, key_seeds=key_seeds,
                   sides=sides, problem_terms=terms)


def solver_fields(config: dict, n: int) -> dict:
    """The pinned solver parameters as the program's solver fields; the
    support size is ``s_per_n`` times the larger side."""
    fields = {k: v for k, v in config["solver"].items()
              if k not in ("family", "s_per_n", "loss")}
    if "s_per_n" in config["solver"]:
        fields["s"] = int(config["solver"]["s_per_n"]) * n
    return fields


# ---------------------------------------------------------------------------
# the program, driven from the client's side
# ---------------------------------------------------------------------------

class Client:
    """Builds request i of a traffic and keeps the geometries on the
    device, as a client that reuses its own objects would."""

    def __init__(self, cell: Cell, traffic: Traffic):
        import jax
        import jax.numpy as jnp
        import repro

        self.cell, self.traffic = cell, traffic
        self._repro, self._jax = repro, jax
        self.geoms = [_geometry(repro.Geometry, jnp, side)
                      for side in traffic.sides]
        self._family = repro.get_solver(cell.config["solver"]["family"])
        self._solvers: Dict[int, Any] = {}

    def solver(self, n: int):
        if n not in self._solvers:
            self._solvers[n] = self._family(
                **solver_fields(self.cell.config, n))
        return self._solvers[n]

    def request(self, i: int):
        """(problem, solver, key) of request i."""
        return self.make(self.traffic.pair_of(i), self.traffic.key_seed(i))

    def make(self, pair: tuple, key_seed: int):
        ix, iy = pair
        problem = self._repro.QuadraticProblem(
            self.geoms[ix], self.geoms[iy],
            loss=self.cell.config["solver"]["loss"], validate=False,
            **self.traffic.problem_terms)
        key = (self._jax.random.PRNGKey(key_seed)
               if getattr(self._family, "requires_key", False) else None)
        return problem, self.solver(max(problem.shape)), key


def _geometry(Geometry, jnp, side: dict):
    """The program's Geometry of one side: from its relation matrix, or
    from its points where it has none; features and points ride along."""
    arrays = {k: jnp.asarray(v) for k, v in side.items()}
    if "relation" not in arrays:
        return Geometry.from_points(arrays["points"], arrays["weights"],
                                    features=arrays.get("features"),
                                    validate=False)
    return Geometry(arrays["relation"], arrays["weights"],
                    features=arrays.get("features"),
                    points=arrays.get("points"), validate=False)


@dataclasses.dataclass
class Done:
    index: int                    # request number in the traffic
    submitted: float              # host clock, seconds
    finished: float
    result: Any                   # RequestResult, or None on error
    error: Optional[BaseException] = None
    admitted: float = float("nan")  # host clock when ``submit`` returned


def serve_config(traffic: dict):
    from repro.serve import ServeConfig
    return ServeConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in traffic.get("serve", {}).items()})


def warm_up(server, client: Client, cell: Cell) -> None:
    """Run every executable the window can use once: for each bucket
    signature of the pool's pairs, one dispatch at every lane width the
    loop can produce; then one request for each geometry size not yet
    seen, for the per-shape host ops of admission (validation, padding).

    The flush timer is held off while warming (``max_wait_s`` raised on a
    copy of the server's config, restored after), so each group goes out
    whole on an explicit flush and the warm-up does the same work every
    run."""
    from repro.obs.span import clear_spans, spans
    from repro.serve.batching import bucket_for

    cfg = server.config
    widths = cell.loop.widths(cell.traffic, cfg)
    sizes = [len(w) for _, w in client.traffic.geoms]
    seen = {}
    for ix, iy in client.traffic.pairs:
        m, n = sizes[ix], sizes[iy]
        sig = (bucket_for(m, cfg.buckets), bucket_for(n, cfg.buckets),
               repr(client.solver(max(m, n))))
        seen.setdefault(sig, (ix, iy))
    server.config = dataclasses.replace(cfg, max_wait_s=3600.0)
    try:
        for pair in seen.values():
            for w in widths:
                clear_spans()
                rids = [server.submit(*client.make(pair, j))
                        for j in range(w)]
                server.flush()
                server.results(rids)
                if not any(r["name"] == "serve.dispatch"
                           and r.get("lanes") == w for r in spans()):
                    raise RuntimeError(f"warm-up did not dispatch {w} lanes")
        covered = {sizes[i] for pair in seen.values() for i in pair}
        rest = []
        for ix, iy in client.traffic.pairs:
            if sizes[ix] not in covered or sizes[iy] not in covered:
                covered |= {sizes[ix], sizes[iy]}
                rest.append((ix, iy))
        for k in range(0, len(rest), widths[-1]):
            rids = [server.submit(*client.make(pair, 0))
                    for pair in rest[k:k + widths[-1]]]
            server.flush()
            server.results(rids)
    finally:
        server.config = cfg
    clear_spans()


def failed(d: Done) -> bool:
    r = d.result
    return (d.error is not None or r.status_name not in HEALTHY
            or r.failed or r.fell_back or not np.isfinite(r.value))


# ---------------------------------------------------------------------------
# the check: served answers against the plain reference
# ---------------------------------------------------------------------------

AGGREGATE = {
    "max": lambda x: float(max(x)),
    "sum": lambda x: float(sum(x)),
    "median": lambda x: float(np.median(x)),
    "p90": lambda x: float(np.percentile(x, 90)),
}


def sample_for_check(done: List[Done], n: int, seed: int) -> List[Done]:
    ok = [d for d in done if d.result is not None]
    if len(ok) <= n:
        return ok
    pick = np.random.default_rng([seed, 1]).choice(len(ok), n, replace=False)
    return [ok[i] for i in sorted(pick)]


def served_answers(cell: Cell, sample: List[Done]) -> List[tuple]:
    """[(request number, what the program said)], on the host."""
    return [(d.index, cell.family.served(d.result)) for d in sample]


def compared_numbers(cell: Cell, traffic: Traffic, checked: List[tuple],
                     names, answers: Optional[list] = None
                     ) -> Dict[str, float]:
    """The numbers ``names`` (of the family's ``NUMBERS``) for the answers
    to the checked requests -- the served ones in ``checked`` = [(request
    number, answer)] unless ``answers`` gives others -- against the
    float32 reference."""
    import jax.numpy as jnp

    if not checked:
        return {}
    fam, solver = cell.family, cell.config["solver"]
    refs = fam.reference(traffic, checked, solver, jnp.float32)
    answers = answers if answers is not None else [a for _, a in checked]
    per = [fam.measures(traffic, i, ans, ref, solver)
           for (i, _), ans, ref in zip(checked, answers, refs)]
    out = {}
    for name in names:
        measure, how = fam.NUMBERS[name]
        out[name] = AGGREGATE[how]([p[measure] for p in per])
    return out


def control_answers(cell: Cell, traffic: Traffic,
                    checked: List[tuple]) -> list:
    """The control: the reference in the program's place, computed one
    precision below the configuration's (float32 -> bfloat16), on the same
    requests; its answers in the served answers' form."""
    import jax.numpy as jnp

    return cell.family.reference(traffic, checked, cell.config["solver"],
                                 jnp.bfloat16)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, number, limit)]): every limit of the cell must
    have its number, within the limit."""
    rows = [(k, numbers.get(k, float("nan")), float(lim))
            for k, lim in limits.items()]
    ok = all(np.isfinite(x) and x <= lim for _, x, lim in rows)
    return ok, rows


def add_src_to_path(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"gwbench: no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
