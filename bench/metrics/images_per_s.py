"""images_per_s: images completed in the window over the window's length.

The window closes at the first step boundary at or after ``--seconds``, so
every image counted finished inside it and no step is cut in two.
"""


def read(rec):
    return rec["images"] / rec["window_s"] if rec["window_s"] > 0 else None
