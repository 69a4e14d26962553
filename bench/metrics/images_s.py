"""Images classified in the window (batches that returned before it
closed) per second of the window."""
from bench.vit_window import window_images


def read(rec):
    return sum(window_images(rec)) / rec.window_s
