"""Compile events the program's ledger (``profiler/ledger.py``) recorded
inside the window; expected 0."""


def compute(ctx):
    return ctx["counters"].get("steady_compiles")
