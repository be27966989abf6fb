"""The benchmark's own code: everything that measures, and nothing of
the program. Only `mirror_child.py` imports `thinvids_tpu` (it runs the
program's XLA mirror in a CPU child); the parent process drives the
daemon over HTTP and never imports jax."""
