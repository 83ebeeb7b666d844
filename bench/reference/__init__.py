"""Plain float32 references in ``jax.numpy``, independent of the program.

Each takes weights by the path names of the program's parameter tree (the
benchmark makes those arrays from the seed; see ``bench/weights.py``) and the
configuration file's published keys, and computes at
``Precision.HIGHEST``.  ``lowp.py`` holds the control's float8 matmul.
"""
