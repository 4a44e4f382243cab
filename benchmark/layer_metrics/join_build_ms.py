"""Launch and transfer: ``join_build_ms``, what a statement spends making
its lookup joins' build sides on the host: the self-time of its
``cop.join_build`` spans (ms; the launches that fetch a build side's rows
are children and are not counted: dedup, scatter or sort, pack, upload
are), median per class, geometric mean over classes.  The reading
``span_self_ms.cop.join_build`` gives, under a name of this cell's own:
that entry lists ``tpch1x1.partjoin`` and may not be edited.  A kept
build's span is its lookup (0.05 ms); one made anew by every statement is
what this metric is for."""

import importlib.util
import os


def read(run, arg=None):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "span_self_ms.py")
    spec = importlib.util.spec_from_file_location("bench_span_self_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run, "cop.join_build")
