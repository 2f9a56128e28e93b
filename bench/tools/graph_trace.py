"""``program_trace.py`` for a configuration given as a layer graph: the
same traced run and readings, with device seconds per stage scope.

    python bench/tools/graph_trace.py --workload yolov3-416-backlog --seed 7

The served bundle runs each node under its stage's scope
(``backbone.s1`` .. ``backbone.s5``, ``neck.13``/``neck.26``/``neck.52``),
then ``decode``, ``nms`` and ``wire``; this tool maps the trace's ops to
those scopes, where ``program_trace.py`` maps them to the configuration's
``layers``, which a graph configuration leaves empty. Arguments as
``program_trace.py``.
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.core import program  # noqa: E402
from bench.tools import program_trace  # noqa: E402


def stage_scopes(cfg: dict) -> tuple:
    """The graph's stage scopes, then post-processing."""
    from bench.systems.yolov3 import program_graph
    return (tuple(scope for scope, _ in program_graph(cfg).stages)
            + program.POST_SCOPES)


if __name__ == "__main__":
    program.layer_scopes = stage_scopes
    program_trace.main()
