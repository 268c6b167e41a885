"""``serve.filled_lane_share``, read in the all-pairs cells, where it moves
``pairs_per_s``."""
import harness

_BASE = harness.load_layer_metric("serve.filled_lane_share")
UNIT, LAYER, read = _BASE.UNIT, _BASE.LAYER, _BASE.read
MOVES = "pairs_per_s"
