"""What a turn of the engine's loop spends under none of its phases: the
window's (d`loop.wall_s` - d sum of `step_phase_seconds`) over its turns.
The account of `step_phase_seconds` is as good as this is small."""
from benchmark import gap_account


def read(observed):
    found = gap_account.window_stats(observed, "loop")
    phases = gap_account.window_stats(observed, "step_phase_seconds")
    turns = gap_account.turns(observed)
    if found is None or phases is None or turns is None:
        return None
    n = sum(t["count"] for t in turns[0].values())
    if n <= 0:
        return None
    wall = found[0]["wall_s"] - found[1]["wall_s"]
    spent = {k: v - phases[1].get(k, 0.0) for k, v in phases[0].items()}
    print(f"[loop] wall {wall:.4f} s, phases {sum(spent.values()):.4f} s "
          f"({', '.join(f'{k} {v:.4f}' for k, v in spent.items())}), "
          f"under no phase {wall - sum(spent.values()):.4f} s over {n} "
          f"turns", flush=True)
    return 1e3 * (wall - sum(spent.values())) / n
