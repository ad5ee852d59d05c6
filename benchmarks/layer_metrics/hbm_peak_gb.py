"""Device memory: the allocator's `peak_bytes_in_use` plus the train step's
`memory_analysis()` temporaries, in GB. On the v5e's runtime the allocator's
counter holds buffers only: the state and every batch the host has placed
ahead of the device (with 171 MB batches that is most of it). A program's
temporaries are not in it (the sequence step holds 11.0 GB of them while the
counter peaks at 0.5 GB), and they are there whenever a step runs, so the
chip's peak is the sum. Both parts are printed on an earlier line."""


def read(run):
  memory = run.get("memory")
  if not memory:
    return None
  return (memory["peak_bytes_in_use"] + memory["step_temp_bytes"]) / 1e9
