"""Plain float32 references of the architectures the program runs."""
