"""Share of the measured window inside admitting turns: the sum of the llm.admit_wave spans over the window's seconds, from the flight recorder alone, so it covers the whole window where the profiler holds a few seconds of it. No decode step is launched meanwhile, so this is the share of the window the running rows stand still for admissions (it includes the wait for the step in flight, which inflight_age_ms on the span sizes)."""

from benchmarks import launch_pairs

read = launch_pairs.wave_time_pct
