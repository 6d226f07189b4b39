"""Plain references the answers are compared with."""
