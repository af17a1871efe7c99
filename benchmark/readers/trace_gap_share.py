"""Share of the traced window in which the device was idle AND the gap is
attributed (tracereduce.attribute_gaps) to one of the program's own spans:
seconds of `idle_gaps` whose label starts with one of `prefixes` and is not
in `except`, over the window, in percent. None where no gap carries such a
label at all — a CPU trace, or a program that writes no spans."""


def read(ctx: dict, prefixes: list, **args):
    t = ctx["trace"]
    if not t or not t.get("window_s"):
        return None
    mine = [(label, s) for label, s in t.get("idle_gaps", ())
            if label.startswith(tuple(prefixes))]
    if not mine:
        return None
    skip = set(args.get("except", ()))
    return 100.0 * sum(s for label, s in mine
                       if label not in skip) / t["window_s"]
