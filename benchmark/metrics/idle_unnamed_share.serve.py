"""Share of the replica's device idle time (gaps between device
operations in the traced window) over whose middle no span of the
program (``rtpu.*`` on ``/host:CPU``) lies. Near zero when the engine's
spans and the device trace share a clock and every phase of a tick is
named, and zero where the device never idles. Nothing where the program
has no such span.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import scopes


def read(obs):
    r = scopes.for_obs(obs)
    if not r or not r["program_spans"] or not r["busy_s"]:
        return None
    return 100.0 * r["idle_unnamed_s"] / r["idle_s"] if r["idle_s"] else 0.0
