"""`calibrate.py` for a cell of the `trainer_streamed` driver: the same
readings by the same code, with the stand-ins' comparison taken leaf by leaf
(`drivers/trainer_streamed.training_numbers`) and the control and the fault
read one after the other against a float32 reference brought to the host,
which is what lets them be read at a size whose parameters fit the host
neither six times over in float64 nor the device three references at once.

    python3 benchmarks/calibrate_streamed.py --workload <cell> --seeds 12 --controls 3
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks import calibrate  # noqa: E402
from benchmarks.drivers import trainer  # noqa: E402
from benchmarks.drivers import trainer_streamed  # noqa: E402


def stand_in_readings(cell, seed: int, rehearse: bool, batches) -> dict:
  """`calibrate.stand_in_readings`, one stand-in at a time."""
  import jax

  reference = cell.reference()
  sizes = trainer.reference_sizes(cell, rehearse)
  truth = jax.device_get(reference.train_steps(seed, sizes, batches))
  rows = len(next(iter(batches[0].values())))
  out = {}
  for name, how in (
      ("control", {"precision": cell.config["control_precision"]}),
      ("half_batch", {"rows": slice(0, rows // 2)})):
    if name == "half_batch" and rows < 2:
      # a batch of one row has no half: nothing to read at this size
      out[name] = {k: float("nan") for k in out["control"]
                   if not k.endswith(".leaves")}
      continue
    stand_in = reference.train_steps(seed, sizes, batches, **how)
    out[name] = calibrate.values(
        trainer_streamed.training_numbers(stand_in, truth))
    del stand_in
  return out


if __name__ == "__main__":
  calibrate.stand_in_readings = stand_in_readings
  sys.exit(calibrate.main())
