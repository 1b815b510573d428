"""Share of the traced window in which no operation ran on the device: 1 - busy / window."""

from benchmarks import stats

read = stats.device_idle_pct
