"""From the start of the step's root span to the start of the job's first
training program on the device's ``XLA Modules`` line, in seconds, mean over
the jobs traced: what a job spends before the device trains.  An earlier line
says which spans that time was spent innermost in (own time, children taken
out), and how much of it only a container's name covers."""

from .. import spans as S


def read(summary, ctx, root, pattern, containers=()):
    spans = S.of(ctx)
    if summary is None or not summary.planes:
        return None
    starts = sorted(s for _, _, s, _ in summary.module_events(pattern, summary.planes[0]))
    heads = []
    for job in S.named(spans, root):
        first = next((t for t in starts if job.start_ns <= t <= job.end_ns), None)
        if first is None:
            continue
        heads.append((first - job.start_ns) / 1e9)
        rows = S.seconds_by_name(S.descendants(spans, job), [(job.start_ns, first)])
        bare = sum(t for n, t in rows if n in containers)
        ctx.say(f"job_head_s {heads[-1]:.3f} = " +
                " + ".join(f"{n} {t:.3f}" for n, t in rows if n not in containers) +
                f"; named {heads[-1] - bare:.3f}, in a container only {bare:.3f} " +
                str({n: round(t, 3) for n, t in rows if n in containers}))
    return sum(heads) / len(heads) if heads else None
