"""Of the window's decode steps, the share whose one-step recurrence (a
Mamba-2 mixer's state update and read-out) ran the Pallas kernel
(`ray_tpu/ops/ssm_step.py`: one pass over a layer's state, where it lies)
and not the jnp form, which XLA makes two fusions and three passes: 100 x
kernel launches / decode steps, between the first and the last of the
window's polls of `engine_stats()`. `state["kernel_steps"]` counts the
decode programs launched whose recurrence the kernel did,
`state["decode_steps"]` every decode program launched by its rows; both
are written at the launch, so the share is exact. Which path a program
takes is the code's choice from what it can see
(`ssm_step.steps_by_kernel`), so a cell reads 100 or 0. A program older
than the counter, or a family with no such state, has no such key and this
gives None."""


def _counts(stats):
    state = stats.get("state") or {}
    if "kernel_steps" not in state or "decode_steps" not in state:
        return None
    return state["kernel_steps"], sum(state["decode_steps"].values())


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
    print(f"[ssm] decode steps in the window: {steps} launched, {kernel} "
          f"with the recurrence stepped by the kernel", flush=True)
    return 100.0 * kernel / steps
