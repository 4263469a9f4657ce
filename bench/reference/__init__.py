"""Plain references of each configuration."""
