"""h2d_share: percent of the window the engine spent in the jnp.asarray
calls that copy each padded flush batch to the device, from the
program's per-flush counters (h2d_s) summed over the window's
StageStats. A program without the counters gives no reading."""


def read(w):
    flush = [getattr(s, "flush", None) for s in w.stage_stats]
    if not flush or None in flush or not w.seconds:
        return None
    return 100.0 * sum(f.h2d_s for f in flush) / w.seconds
