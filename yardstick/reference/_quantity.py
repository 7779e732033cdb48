"""Kubernetes resource quantities to integers: cpu in millicores, anything
else in whole units (bytes, pods)."""

from fractions import Fraction

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "Pi": 2 ** 50, "Ei": 2 ** 60, "n": Fraction(1, 10 ** 9),
           "u": Fraction(1, 10 ** 6), "m": Fraction(1, 1000),
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9, "T": 10 ** 12,
           "P": 10 ** 15, "E": 10 ** 18}


def parse(text) -> Fraction:
    s = str(text).strip()
    for suffix in sorted(_SUFFIX, key=len, reverse=True):
        if s.endswith(suffix):
            return Fraction(s[:-len(suffix)]) * _SUFFIX[suffix]
    return Fraction(s)


def amount(resource: str, text) -> int:
    """Requests round up, as the scheduler counts them."""
    q = parse(text) * (1000 if resource == "cpu" else 1)
    return -((-q.numerator) // q.denominator)
