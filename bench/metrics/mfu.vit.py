"""Model operations of the images classified in the window (ViT-B/16's
forward, 35.1 GFLOP an image) over the window times the chips' peak."""
from bench import counts
from bench.vit_window import window_images


def read(rec):
    flops = counts.vit_image_flops(rec.data["model"]) * sum(
        window_images(rec))
    peak = counts.peaks(rec.device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (rec.window_s * rec.data["chips"] * peak)
