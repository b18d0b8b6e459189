"""Programs the fused runner compiled inside the window (should be 0)."""


def read(rec):
    return rec.compiles_in_window
