"""Median length of an admitting turn (llm.admit_wave: from the entry of the engine's turn to the return of its admissions, recorded only where the turn launched a prefill program or gave a request a slot) over the measured window. Every running decode stands still for that long, which the worst gaps between streamed tokens read."""

from benchmarks import launch_pairs

read = launch_pairs.wave_ms_p50
