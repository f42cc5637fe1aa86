"""The fullest owner's Newton-Schulz FLOPs over the owners' mean, by the
program's own plan: its gauge ``engine.update.muon.owner_flops`` (x1000,
set by every step under ``muon``; 1000 on one shard) over 1000.  1.0 is a
level deal; the step's products wait for the fullest owner, so
``muon_owned_ns_ms`` is about this times a level deal's.  None on a program
without the gauge or before any step under ``muon``."""


def read(ctx):
    if not ctx.spans:
        return None
    try:
        import pslite_tpu as ps

        engine = ps.postoffice(ps.Role.WORKER).van.engine
        spread = engine.muon_owner_flops
    except (ImportError, AttributeError, KeyError):
        return None
    return spread / 1000.0 if spread else None
