"""output_tok_s: token-bearing events returned in the window, over the
window's seconds (host clock)."""


def read(w):
    return w.tokens() / w.run.window_s if w.run.window_s > 0 else None
