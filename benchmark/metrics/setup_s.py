"""Seconds from process start to the first timed tick: imports, building
the App and sessions from the seed, warming every shape the traffic uses
(compilation or compile-cache loads included)."""


def read(run):
    return run.setup_s
