"""Mean over the traced window's launches of a decode program
of the `args` part of `llm.dispatch` (`benchmark/launch_account.py`): the
interval outside `PjitFunction(...)` (the wrapper) plus `PjitFunction`'s self
time and `ParseArguments`: the tree of arguments flattened and checked, the
signature looked up, the results wrapped. What a call on pre-flattened buffers
or an AOT executable would take out (ROADMAP S6 (c)). Also prints the run's
`[launch]` line."""
from benchmark import launch_account


def read(observed):
    launch_account.report(observed)
    return launch_account.mean_part_ms(observed, "decode", "args")
