"""Shared code of the benchmark: the manifest, the last line, the peaks
table, percentile arithmetic, the open-loop generator and the reduction
from a profiler trace to busy, idle, per-name time and gaps."""
