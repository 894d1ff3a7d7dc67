"""Seconds the saturation optimizer spent building or replaying tile
programs during set-up (the program's telemetry: cold + warm + hit wall
time)."""


def read(view):
    return view.sat_build_s
