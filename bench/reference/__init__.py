"""Plain float32 reference of the PAAC job the benchmark cells run."""
