"""Mean over the traced window's launches of a decode program
of the `put` part of `llm.dispatch` (`benchmark/launch_account.py`): self time
under `DevicePut` and the runtime's transfer events (`launch_account.PUT`) on
the loop thread: the step's host arrays, each its own transfer. What one
packed buffer a step would take out (ROADMAP S6 (e)). `execute` and `unlisted`
are in the `[launch]` line: the launch less `args` less this."""
from benchmark import launch_account


def read(observed):
    return launch_account.mean_part_ms(observed, "decode", "put")
