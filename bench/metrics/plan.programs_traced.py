"""Partition programs jit traced inside the window (layer: plan +
preparation; the growth of the always-on ``programs_traced`` counter). A
warm window reads 0. None for a program without the counter."""


def read(run):
    return run.counters.get("programs_traced")
