"""Host clock around the runner's warm-up (for serving the prewarm ladder): compile, or compile-cache load."""

def read(ctx, name):
    return ctx.phases.get("setup_compile_s")
