"""Plain references: straightforward jax.numpy in float32 at matmul
precision "highest", importing nothing of the program."""
