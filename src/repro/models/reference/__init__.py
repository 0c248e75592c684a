"""Plain float32 references of the registry's architectures, for the tests:
each follows the published equations with no kernel, cache or batching trick."""
