"""FanStore's chip benchmark: one cell of ``BENCHMARK.json`` per run.

The harness owns traffic generation, the reduction from spans and traces
to metrics, the table of peaks, the FLOP counts and the comparison that
decides ``correct``. From the program it takes the data plane, the train
step and the kernels, called as ``launch/train.py`` calls them.
"""
