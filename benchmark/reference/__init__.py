"""Plain references that decide ``correct``."""
