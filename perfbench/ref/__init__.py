"""Plain references: float32 jax.numpy, no kernel, nothing of the program."""
