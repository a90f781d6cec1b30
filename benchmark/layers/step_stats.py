"""Shared by the readers of ``LMEngine``'s step-phase counters
(``step_host_ms_per_chunk``, ``decode_wait_ms_per_step``, ``admit_stall_ms``,
``admission_wait_ms``, ``step_longest_ms``, ``engine_first_use_s``): differences of
``LMEngine.stats`` between the window's start and its close. An engine
without the counters (a commit before the spans) gives None, and so does
a base of 0."""


def per(ctx, plus, minus, base):
    """(sum of the differences of ``plus`` - those of ``minus``) over the
    difference of ``base``; the counters are keys of ``LMEngine.stats``."""
    a, b = ctx.window.stats_start, ctx.window.stats_end
    if any(k not in a or k not in b for k in (*plus, *minus, base)):
        return None
    n = b[base] - a[base]
    if n <= 0:
        return None
    return (sum(b[k] - a[k] for k in plus)
            - sum(b[k] - a[k] for k in minus)) / n
