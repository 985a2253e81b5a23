"""Chip benchmark of the serve path.

``python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything a cell uses is found by name under this directory:

* ``configs/<config>.json``: the model configuration as it is run, with its
  source, the keys cut from it and the deployment (slots, max_len, pages);
* ``traffic/<mix>.json``: the parameters of a traffic mix, read by the one
  general generator in ``traffic.py``;
* ``metrics/<metric>.py``: one reader per metric, which takes its number
  from the run's stamps, counters or reduced trace;
* ``work/<family>.py``: the operations and bytes a step needs;
* ``reference/<family>.py``: the plain float32 reference and the seeded
  weights it shares with the served model.

A new cell, configuration, mix or metric is a new file and an entry in
``BENCHMARK.json``; no existing file changes.
"""
