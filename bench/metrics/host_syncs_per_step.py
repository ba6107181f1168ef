"""Device values the program's ``Trainer`` turned into Python values
(``Trainer.host_syncs``, each a wait for the device) per training step of
the window: a wait a change adds to the loop shows here."""


def read(out):
    syncs = out.counters.get("program.host_syncs")
    steps = out.counters.get("steps")
    if syncs is None or not steps:
        return None
    return syncs / steps
