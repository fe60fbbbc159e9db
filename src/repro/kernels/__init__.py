"""repro.kernels — the Pallas K1 contact-force kernel (collision_force.py),
its jit'd wrappers (ops.py) and its dense oracle (ref.py)."""
