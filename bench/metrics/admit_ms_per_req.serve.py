"""Host milliseconds of the engine ticks that admitted requests
(scheduling, padded prefill, the cache writes of ``write_prompt``, and
that tick's decode step), over the requests they admitted."""


def read(run):
    ticks = [(e - s, a["admitted"]) for n, s, e, a in run.spans.rows
             if n == "tick" and a.get("admitted") and s >= run.window_t0]
    admitted = sum(k for _, k in ticks)
    return 1e3 * sum(d for d, _ in ticks) / admitted if admitted else None
