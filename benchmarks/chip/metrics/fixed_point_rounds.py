"""Rounds the closed fixed point took to converge (the largest over the
window's sweeps); nothing for an open-loop cell."""


def read(run):
    rounds = [s["rounds"] for s in run["sweeps"] if s["rounds"] is not None]
    return max(rounds) if rounds else None
