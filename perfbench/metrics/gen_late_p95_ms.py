"""How late the generator ran: 95th percentile of (actual send - due). A
starved generator must not read as a fast server."""

def read(ctx):
    gen = ctx.get("generator")
    return gen.get("late_p95_ms") if gen else None
