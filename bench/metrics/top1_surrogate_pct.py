"""Mean over answered requests of the top-1 surrogate of the sub-network
that served each one, from its exact FLOPs over full width's."""
from bench import stats


def read(run):
    w = run.window
    full = run.flops(run.family.FULL)
    acc = {}
    vals = []
    for k, name in enumerate(w.subnet):
        if w.error[k] is not None:
            continue
        if name not in acc:
            acc[name] = stats.top1_surrogate(run.flops(name) / full)
        vals.append(acc[name])
    return sum(vals) / len(vals) if vals else None
