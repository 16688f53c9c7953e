"""How much of the window the stalls ate: ``waited_ms - usual_ms`` of the
``rtpu.train.stall`` events that start in the window at the loop's main
place (its ``block_until_ready`` line; a compile is a long wait of
another place), over the window. 0 in a clean run. In a traced run of a cell whose profiler runs inside
the window, the profiler's own long step is among them.
source: program_span (trace_spans.json, lib/loop_spans.py)."""
from benchmark.lib import loop_spans


def read(obs):
    return loop_spans.window_share(
        obs, loop_spans.STALL, lambda a: a["waited_ms"] - a["usual_ms"],
        at_main_place=True)
