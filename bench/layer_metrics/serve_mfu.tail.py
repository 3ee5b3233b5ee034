"""``serve_mfu`` read in the cell below its knee, where the whole step's
share bounds a claim on ``tpot_p95_ms`` beside ``decode_hbm_roofline``:
the same reduction, under the name of the metric it moves there."""
from bench.harness.manifest import reader

read = reader("serve_mfu").read
