"""Share of the traced window in which no operation ran on the device."""
from harness import readers

read = readers.device_idle_share
