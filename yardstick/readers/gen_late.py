"""How late the sender ran: a percentile of (sent - due) over the groups,
in milliseconds."""

from ..quantiles import percentile


def read(facts, args):
    late = [(g[2] - g[0]) * 1000.0 for g in facts["groups"]
            if g[2] is not None]
    return percentile(late, args["q"]) if late else None
