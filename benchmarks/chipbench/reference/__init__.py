"""Plain references of the configurations' architectures (float32 jax.numpy,
no kernels, cache or batching); a configuration names its own by file name."""
