"""The chip benchmark: one cell (a model configuration under a traffic
mix) run once per process on a TPU.

    python3 -m bench.run --workload W --seed N --seconds S --trace 0|1

Everything that belongs to one configuration, block, traffic mix, cell
or per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``    model sizes, dtype, source, cuts, and
                                     the name of its block
- ``bench/blocks/<block>.py``        one kind of layer: its program
                                     configuration, weights, reference and
                                     operation counts
- ``bench/traffic/<traffic>.json``   parameters of the load generator
- ``bench/cells/<workload>.json``    engine settings and correctness limits
- ``bench/metrics/<metric>.py``      a reader of one per-layer metric

The yardstick (load generation, the reduction of profiler traces, the
table of peaks, the roofline, the float32 reference around a block's
layers and the comparison that decides ``correct``) lives here and
imports nothing of the program under test; only ``bench/harness.py``
drives the program.
"""
