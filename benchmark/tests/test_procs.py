"""``lib/procs.wait_for_children``: a run ends only when every process
it started has ended."""
import subprocess
import sys
import time

from benchmark.lib import procs


def test_waits_for_a_child_nobody_reaped():
    subprocess.Popen([sys.executable, "-c", "import time; time.sleep(0.4)"])
    t0 = time.monotonic()
    ended = procs.wait_for_children(grace_s=10)
    assert ended["reaped"] == 1 and ended["killed"] == 0
    assert 0.2 < time.monotonic() - t0 < 5
    assert procs.wait_for_children()["reaped"] == 0


def test_kills_a_child_that_outlives_the_grace():
    subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    ended = procs.wait_for_children(grace_s=0.3)
    assert ended["reaped"] == 1 and ended["killed"] == 1
    assert ended["seconds"] < 5


def test_host_memory_share_is_a_share():
    assert 0.0 <= procs.host_memory_used_share() < 1.0
