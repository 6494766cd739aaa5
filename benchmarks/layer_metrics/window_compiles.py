"""device: programs compiled inside the window ([stats] device.compiles delta); set-up left undone (count)."""
from benchmarks.harness import readers

read = readers.window_compiles
