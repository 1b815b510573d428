"""Median idle time of the device around an admitting turn's prefills, over the waves of the traced seconds: from the end of the last decode run before a wave's first prefill run to the start of the first decode run after its last, less the programs that ran in between. The runs are found by the launch numbers the spans carry (seq), not by the clock anchor."""

from benchmarks import launch_pairs

read = launch_pairs.admit_device_idle_ms_p50
