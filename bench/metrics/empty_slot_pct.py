"""Share of pipeline slots that ran empty over the window: 1 - injected
microbatches / (ticks x replicas), from the server's ``run()`` counters.
Reads ``empty_slot_pct.stream`` and ``empty_slot_pct.alone`` alike."""


def read(run):
    c = run["counters"]
    slots = c["ticks"] * c["replicas"]
    return 100.0 * (1.0 - c["injected"] / slots) if slots else None
