"""A run waits for the processes it started until they have really ended."""

import subprocess
import sys
import time

from benchmarks import harness

# The first thread ends alone (the exit system call, not exit_group), so the
# process is a zombie whose second thread lives on for a while: what a killed
# worker looks like while the kernel takes its mappings apart.
THREADED_ZOMBIE = """
import ctypes, os, threading, time
threading.Thread(target=lambda: (time.sleep({linger}), os._exit(0))).start()
print("up", flush=True)
ctypes.CDLL(None).syscall({sys_exit}, 0)
"""


def test_a_zombie_whose_threads_still_run_has_not_ended():
    import platform

    sys_exit = {"x86_64": 60, "aarch64": 93}.get(platform.machine())
    if sys_exit is None or not sys.platform.startswith("linux"):
        import pytest

        pytest.skip("needs the number of the exit system call")
    child = subprocess.Popen(
        [sys.executable, "-c", THREADED_ZOMBIE.format(linger=1.5, sys_exit=sys_exit)],
        stdout=subprocess.PIPE,
    )
    assert child.stdout.readline().strip() == b"up"
    time.sleep(0.3)
    table = harness._proc_table()
    assert table[child.pid][1] == "Z"
    assert not harness._ended(child.pid, table)
    t = time.time()
    harness.wait_until_ended({child.pid})
    assert time.time() - t > 0.8
    assert child.pid not in harness._proc_table()  # reaped: all of it is gone


def test_another_process_child_is_judged_by_proc():
    """A grandchild cannot be reaped from here; /proc has to say it ended."""
    outer = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "c = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(1.0)'])\n"
         "print(c.pid, flush=True)\ntime.sleep(3)"],
        stdout=subprocess.PIPE,
    )
    inner = int(outer.stdout.readline())
    assert not harness._ended(inner, harness._proc_table())
    t = time.time()
    harness.wait_until_ended({inner})  # ends as an unreaped zombie of `outer`
    assert 0.3 < time.time() - t < 2.5
    outer.kill()
    outer.wait()


def test_an_ended_process_is_not_waited_for():
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    while child.pid in harness._proc_table() and harness._proc_table()[child.pid][1] != "Z":
        time.sleep(0.05)
    t = time.time()
    harness.wait_until_ended({child.pid})  # a zombie with no thread left
    assert time.time() - t < 1.0
    child.wait()
