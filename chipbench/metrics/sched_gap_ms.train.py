"""Mean host time between one iteration's end and the next one's start, in
the window, less the time inside ``Session.place``/``release`` in that
gap. Every trainer is runnable throughout, so each gap is the executor's
own time: its pick, policy and memory tick. Layer: the executor."""


def read(run):
    its = sorted(run.spans.of("run_iteration"), key=lambda s: s.t0)
    moves = run.spans.of("place") + run.spans.of("release")
    gaps = []
    for a, b in zip(its, its[1:]):
        inside = sum(min(m.t1, b.t0) - max(m.t0, a.t1) for m in moves
                     if m.t1 > a.t1 and m.t0 < b.t0)
        gaps.append((b.t0 - a.t1 - inside) * 1e3)
    return sum(gaps) / len(gaps) if gaps else None
