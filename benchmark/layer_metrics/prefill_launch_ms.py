"""The jitted call of a prompt's or a chunk's program, alone: the window's
d`launch["prefill"].wall_s` / d`calls` of `engine_stats()`, two `perf_counter`
readings inside the runner's lock around the call and nothing else
(`ModelRunner.launch_*`), so that `llm.dispatch` less this is the wrapper.
Over the whole window, of which the profiler is on for the last seconds only:
the `[launch]` line gives the two apart. What one packed host buffer a step
and pre-flattened arguments would take out together (ROADMAP S6 (e), (c)). A
program without the counter gives None."""
from benchmark import launch_account


def read(observed):
    return launch_account.launch_ms(observed, "prefill")
