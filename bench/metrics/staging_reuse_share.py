"""staging_reuse_share: percent of the window's batch loads that the
engine assembled in host staging buffers it already had, neither
allocating nor growing one, from the program's per-flush counters:
100 x Σstaged_reuses / Σ(staged_reuses + staged_allocs) over the window's
StageStats. A program without the counters, or a window that loaded no
batch, gives no reading."""


def read(w):
    counts = [(getattr(f, "staged_reuses", None),
               getattr(f, "staged_allocs", None))
              for f in (getattr(s, "flush", None) for s in w.stage_stats)]
    if not counts or any(None in c for c in counts):
        return None
    reuses = sum(r for r, _ in counts)
    loads = reuses + sum(a for _, a in counts)
    return 100.0 * reuses / loads if loads else None
