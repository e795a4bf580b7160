"""Plain references: float32 ``jax.numpy`` models that decide ``correct``."""
