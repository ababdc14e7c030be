"""One module per kind of job. A pipeline builds, for one configuration
under one traffic mix, the store, the program's reader, loader and jitted
step (the timed path), and the plain reference that follows it. The
configuration's file names its pipeline; ``common.Job`` is what the runner
drives."""
