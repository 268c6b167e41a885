"""``serve.collect_ms_per_solve``, read in the all-pairs cells, where it moves
``pairs_per_s``."""
import harness

_BASE = harness.load_layer_metric("serve.collect_ms_per_solve")
UNIT, LAYER, read = _BASE.UNIT, _BASE.LAYER, _BASE.read
MOVES = "pairs_per_s"
