"""One driver per entry point of the program: ``run(run: harness.Run)``."""
