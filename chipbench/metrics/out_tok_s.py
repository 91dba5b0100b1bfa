"""Output tokens delivered inside the window, over the window's
seconds (the drain after it is not counted)."""


def read(run):
    w = run.window
    return sum(s.n_out_window for s in w.served) / w.seconds
