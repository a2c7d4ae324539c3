"""Plain references: straightforward jax.numpy in float32 at ``highest``
precision, no kernels, no cache, no batching tricks; they import nothing
of the program."""
