"""The suite runs on the CPU, where the Pallas kernels can only run in the
interpreter: ask for it here (``repro.kernels.interpret_mode``)."""
import os

os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
