"""Programs jax compiled inside the measured window; expected 0."""
from harness import readers

read = readers.compiles_in_window
