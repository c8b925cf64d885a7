"""Plain references that decide ``correct``.

They import nothing of the program: the sizes come from the cell's
config file, the weights from the benchmark's own initialisation
(``bench/weights.py``), laid out as the program's parameter tree and read
by name.  Every matmul runs in float32 at ``HIGHEST`` precision; with
``fp8=True`` its operands are first rounded to float8 (e4m3 forward,
e5m2 backward, one amax scale per tensor) — the control, one precision
below the bfloat16 the configurations state.
"""
