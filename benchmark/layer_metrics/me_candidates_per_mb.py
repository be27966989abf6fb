"""ME kernel: the candidates the motion search of the executable that
ran scores per macroblock: the gauge `me_candidates` of `stage_ms` at
the window's last snapshot (set at every call of a GOP or step program
that searches motion; 227 at subpel=half, 379 at quarter). Every one
of them is a window of the reference laid against the macroblock and
block-summed on the matrix unit, so the kernel's time goes with it.
Not measured where the program has no such gauge."""


def read(ev):
    candidates = ev["snapshot"]["after"].get("me_candidates")
    return None if candidates is None else float(candidates)
