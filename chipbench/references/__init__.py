"""Plain references, one module per app, independent of the compiler.

Each module gives ``reference(inputs, xp=numpy, dtype=numpy.float64)``:
``inputs`` maps the app's input names to arrays in loop order (an image is
indexed ``[y, x]``; ``f[x, y]`` in the app DSL reads ``a[y, x]``).  Every
operation runs in ``xp`` on arrays of ``dtype``, so the same code gives the
float64 reference (``numpy``) and the lower-precision control
(``jax.numpy`` in bfloat16, where each eager op rounds its result).
"""

import importlib


def load(name: str):
    """The reference module named by a configuration's ``reference``."""
    return importlib.import_module(f"{__name__}.{name}")
