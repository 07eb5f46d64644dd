"""Shared access of the readers of the program's own spans:
``trace/program_spans.py`` loaded once per process, so that its parse of a
run's trace serves every reader."""

import sys

import harness

NAME = "bench_trace_program_spans"


def module():
    return (sys.modules.get(NAME)
            or harness.load_module(harness.BENCH / "trace" /
                                   "program_spans.py", NAME))
