"""Plain float32 references of the served architectures, independent of
the program under test, and the comparison that decides ``correct``."""
