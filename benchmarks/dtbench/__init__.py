"""The benchmark's own library: traffic, client, arithmetic, reference,
trace reduction. `run.py` is the entry point; nothing here imports the
program, and only `reference.py` and `trace_reduce.py` import JAX (each
runs as a child process of its own)."""
