"""Tokens generated by the pool members over all legs, over the whole
window. A generate call that the window's close cuts counts with the
share of its time that lay inside the window: its tokens come back at
its end, but its work was spread over it."""


def read(run):
    close = run.window.t_close
    tokens = 0.0
    for g in run.rec.gens:
        inside = min(g["t1"], close) - g["t0"]
        if inside > 0:
            tokens += (sum(len(o) for o in g["outs"]) * inside
                       / max(g["t1"] - g["t0"], 1e-9))
    return tokens / close if tokens else None
