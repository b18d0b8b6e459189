"""Seconds from the start of the process to the opening of the window:
imports, world and estimator set-up, warming every batch bucket (compile
or compile-cache load), and the fill."""


def read(rec):
    return rec.setup_s
