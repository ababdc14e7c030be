"""One reader per per-layer metric; see README.txt."""
