"""Seconds from the command's start to the window's: imports, weights,
engine or arena, warming every shape the cell uses (compile or cache
load), and reaching the cell's steady state (host clock)."""


def read(run):
    return run["setup_s"]
