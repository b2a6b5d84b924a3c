"""Share of the traced window in which the chips ran the lane window
program (_window_exec) on the mesh: read as window_kernel_share.storm
reads it, the module's device time averaged over the chips, over the
window."""

from benchmarks.run import ROOT, metric_reader

read = metric_reader("window_kernel_share.storm", ROOT)
