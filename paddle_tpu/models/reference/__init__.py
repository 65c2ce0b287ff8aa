"""Plain references of the model families: straightforward jax.numpy, float32, no kernels (tests compare the program with them)."""
