"""Share of the traced window in which no operation ran on a chip of
the mesh: read as device_idle.storm reads it, 1 - (union of each
chip's op intervals / window), averaged over the four chips."""

from benchmarks.run import ROOT, metric_reader

read = metric_reader("device_idle.storm", ROOT)
