"""Host clock: corpus / seen lists / factor tables and engine, or their cache load."""

def read(ctx, name):
    return ctx.phases.get("setup_data_s")
