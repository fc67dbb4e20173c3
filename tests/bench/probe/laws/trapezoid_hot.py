"""The trapezoid law with a reference half that states a rate 10 % above
the program's: a reference that disagrees with the program."""
from pathlib import Path

import lookup

_trapezoid = lookup.load(Path(__file__).resolve().parents[1], "laws",
                         "trapezoid")
program = _trapezoid.program


def reference(law: dict, t, q):
    rate, size = _trapezoid.reference(law, t, q)
    return q(1.10 * rate), size
