"""Traffic mixes: one data file of parameters each (``<name>.json``),
driven by the one general generator in ``loop.py``."""
