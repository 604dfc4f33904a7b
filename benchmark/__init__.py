"""The benchmark of eigentrust-tpu: one data-driven harness.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line. Everything the harness needs is found by name:

- ``benchmark/configs/<config>.json``: a deployment (sizes, semantics,
  source, what was cut);
- ``benchmark/traffic/<mix>.json``: a traffic mix, the parameters the
  runner it names reads;
- ``benchmark/runners/<runner>.py``: a general runner (set-up, measured
  window, correctness check) for one kind of traffic;
- ``benchmark/metrics/<metric>.py``: one reader per per-layer metric.

The yardstick lives here too: the seeded Kronecker generator
(``kronecker.py``), the float64 reference and its lower-precision
control (``reference.py``), the device-trace reduction
(``trace_reduce.py``) and the table of peaks with the work counts of
the roofline (``roofline.py``).
"""
