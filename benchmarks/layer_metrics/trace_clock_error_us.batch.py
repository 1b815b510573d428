"""The least error the clock anchor's laying of host spans on the device trace provably has: the largest amount by which a run starts before the dispatch or prefill span that launched it begins, or ends after the read-back of its tokens does, over the spans paired with their runs by launch number. 0 expected; a reading of a millisecond or more means every name in idle_gaps and every run that runs_of_phase picked in this traced run is suspect."""

from benchmarks import launch_pairs

read = launch_pairs.clock_error_us
