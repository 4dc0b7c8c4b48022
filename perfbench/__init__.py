"""The on-chip benchmark: one command, cells found by name from data."""
