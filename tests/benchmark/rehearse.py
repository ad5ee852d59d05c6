"""What the run and fault tests share: the cells, and one rehearsal of
`run.py` in this process."""

import json

from benchmarks import run as run_lib
from benchmarks.harness import manifest

CELLS = [w["name"] for w in manifest.load_benchmark()["workloads"]]


def rehearse(capsys, cell, trace=0, seed=2_147_483_700):
  code = run_lib.main(["--workload", cell, "--seed", str(seed), "--trace",
                       str(trace), "--rehearse"])
  captured = capsys.readouterr()
  assert code == 0
  return json.loads(captured.out.strip().splitlines()[-1]), captured.err
