"""Programs compiled inside the measured window: trips through the backend compile path that the persistent cache did not serve. 0 expected."""

from benchmarks import stats

read = stats.window_compiles
