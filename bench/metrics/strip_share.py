"""Share of patches that took the executor's strip path, over the requests
completed in the window (the executor's own strip and full counters)."""

# last_stats name -> the PlanExecutor attribute it is published from
COUNTERS = {"deep_strip_patches": "_deep_strips", "deep_full_patches": "_deep_fulls"}


def read(run):
    done = {r.rid for r in run.requests.values() if r.done is not None and r.done <= run.t_close}
    ticks = [t for t in run.window_ticks if t.rids and set(t.rids) <= done]
    strips = sum(t.counters["deep_strip_patches"] for t in ticks)
    total = strips + sum(t.counters["deep_full_patches"] for t in ticks)
    return 100.0 * strips / total if total else None
