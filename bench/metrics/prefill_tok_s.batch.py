"""Scheduler's chunked prefill: prompt tokens the window's steps took into
the KV cache (each row's committed positions below its prompt's length,
again after a preemption; prompt tokens the prefix cache supplied are not
computed and not counted), over the window's length (closed-loop cells)."""


def read(run):
    _, steps = run.window_steps()
    if not steps:
        return None
    prompt = {r.req.rid: len(r.prompt) for r in run.record.recs}
    taken = sum(max(0, min(c + n, prompt[rid]) - c)
                for s in steps for rid, c, n in s.rows)
    return taken / run.close
