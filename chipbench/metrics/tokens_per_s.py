"""Tokens served in the window over the window's length (host clock).

The count is the engine's ``n_generated`` counter, read when the window
opens and when it closes."""


def read(m):
    return m.generated / m.window_s if m.window_s > 0 else None
