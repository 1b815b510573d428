"""Median length of the engine's decode step (dispatch, logits read-back, sampling) from its llm.decode_step spans inside the window."""

from benchmarks import stats

read = stats.engine_step_ms_p50
