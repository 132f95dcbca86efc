"""The chip benchmark: named cells of this store on a TPU, run one at a
time by ``run.py``.  See ``BENCHMARK.json`` at the checkout's root."""
