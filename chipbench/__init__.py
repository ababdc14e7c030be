"""The on-chip benchmark of petastorm-tpu. ``python3 -m chipbench.run`` is
the one entry; ``chipbench/README.md`` says what each file is and which are
shared (only a ``benchmark`` PR's to change)."""
