"""Fresh ratings committed per second of the window: the cursor of the last
commit unit published by the window's close, over the window (every rating of
the mix is fresh).  A runner that streams nothing reports nothing."""


def read(ctx, name):
    done = ctx.window.get("ratings_committed_in_window")
    return None if done is None else done / ctx.window["window_s"]
