"""The benchmark: one harness driven by the entries of BENCHMARK.json.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` finds the cell in ``BENCHMARK.json``, its configuration in
``configs/<config>.json``, its traffic in ``traffic/<traffic>.json`` (which
names its driver), its limits in ``limits/<cell>.json`` and each per-layer
metric in ``layer_metrics/<name>.py``. A later PR adds a cell by adding such
files and one entry; no file that is here needs an edit.
"""
