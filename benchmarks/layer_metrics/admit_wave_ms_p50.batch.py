"""Median length of an admitting turn (llm.admit_wave: from the entry of the engine's turn to the return of its admissions, recorded only where the turn launched a prefill program or gave a request a slot) over the measured window, in a cell above the knee: the prefill programs, the wait for the decode step in flight before them, the first samples and the books."""

from benchmarks import launch_pairs

read = launch_pairs.wave_ms_p50
