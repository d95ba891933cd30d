"""A percentile of the load generator's own records (host clock, the
client's side): ``stat`` is ``gen_lag`` (sent - due), ``ttft``,
``tpot`` (one reading a request) or ``itl`` (one a token gap), all in
ms; ``q`` the percentile."""

from typing import Optional

from benchmark import stats


def read(ctx, stat: str, q: float) -> Optional[float]:
    measured = ctx.get("measured")
    if not measured:
        return None
    if stat == "gen_lag":
        values = [1e3 * (r["sent"] - r["due"]) for r in measured]
    elif stat == "ttft":
        values = stats.ttft_ms(measured)
    elif stat == "tpot":
        values = stats.tpot_ms(measured)
    elif stat == "itl":
        values = stats.itl_ms(measured)
    else:
        raise ValueError(f"unknown client statistic {stat!r}")
    return stats.percentile(values, q) if values else None
