"""Shared helpers. The package imports none of its modules, so that the
pipeline's processes can import `tracing` without bringing in JAX."""
