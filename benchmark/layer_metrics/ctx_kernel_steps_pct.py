"""Of the window's decode steps, the share whose read of the lanes' cached
context ran the Pallas kernel (`ray_tpu/ops/paged_attention.py`) and not
the XLA tile loops: 100 x kernel launches / decode steps, between the
first and the last of the window's polls of `engine_stats()`.
`context_by_kind[kv kind][program]["kernel_steps"]` counts the launches
of a decode or verify program whose read of that kind of KV layer the
kernel did (the most of the kinds is taken: a launch is one launch),
`steps["decode"]` the decode steps whose results were read; a step in
flight at a poll is launched and not yet read, so the share may miss 100
by one step in the window's thousands. Which path a program takes is the
code's choice from what it can see (`context_attention.reads_by_kernel`),
so a cell reads 100 or 0. A program older than the counter has no such
key and this gives None."""


def _counts(stats):
    by_kind = stats.get("context_by_kind") or {}
    steps = (stats.get("steps") or {}).get("decode")
    if steps is None or not by_kind or any(
            "kernel_steps" not in by.get("decode", {})
            for by in by_kind.values()):
        return None
    return (max(by["decode"]["kernel_steps"]
                + by.get("verify", {}).get("kernel_steps", 0)
                for by in by_kind.values()), steps)


def read(observed):
    polls = observed.get("polls") or []
    if len(polls) < 2:
        return None
    first, last = _counts(polls[0]), _counts(polls[-1])
    if first is None or last is None:
        return None
    kernel, steps = last[0] - first[0], last[1] - first[1]
    if steps <= 0:
        return None
    print(f"[ctx] decode steps in the window: {steps} read, {kernel} "
          f"launched with the context read by the kernel", flush=True)
    return 100.0 * kernel / steps
