"""Plain float32 references of the model families the cells serve."""
