"""The chip benchmark: cells of (model configuration x deployment x
traffic) run on a TPU, each printing one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Layout, every part found by name from ``BENCHMARK.json``:

* ``configs/<config>.json`` — a model configuration as it is run;
* ``cells/<cell>.json`` — a deployment: the system module that builds it
  (``systems/``), its settings, and the limits of its check;
* ``traffic/<mix>.json`` — a traffic mix, and the generator module that
  drives it (``traffic/<generator>.py``);
* ``metrics/<metric>.py`` — one reader per per-layer metric;
* ``reference/`` — plain references that import nothing of the program;
* ``flops.py``, ``peaks.json``, ``trace.py`` — the benchmark's own
  operation counts, the chips' peaks, and the reduction of a profiler
  trace to busy, idle, op and collective time.

``sweep.py`` finds a serving cell's knee.  ``python -m pytest chipbench``
runs the benchmark's own tests on the CPU.
"""
