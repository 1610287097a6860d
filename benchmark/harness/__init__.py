"""The benchmark's own yardstick: manifest, peaks, operation counts, weights
from a seed, the plain reference, traffic generation, window arithmetic and
the reduction of a profiler trace. Nothing here imports the program."""
