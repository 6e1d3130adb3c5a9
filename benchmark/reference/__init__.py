"""Plain references: ``jax.numpy``, float32, written from the published
descriptions. Nothing here imports the program."""
