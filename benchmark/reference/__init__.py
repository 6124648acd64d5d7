"""Plain references: each architecture's forward pass (and, through
``jax.grad``, its gradients) in straightforward float32 ``jax.numpy``,
written from the published descriptions.  Nothing here imports the package
under test, and nothing here is given anything the program made: weights
come from the benchmark's own seeded draw (``harness/weights.py``), in the
tree layout of that draw."""
