"""The window's batches of the closed-loop ViT client (shared by the
ViT metrics)."""


def window_batches(rec):
    return [b for b in rec.data["batches"] if b["end"] <= rec.data["t_close"]]


def window_images(rec):
    return [b["batch"] for b in window_batches(rec)]
