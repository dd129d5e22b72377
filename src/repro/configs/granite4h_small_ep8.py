"""One chip's share of granite-4.0-h-small in an eight-chip expert group:
``granite4h_small.SHARE`` (layers 0-9, experts 0-8 of 72, an eighth of the
vocabulary) registered as an arch of its own."""
from repro.configs.granite4h_small import SHARE as CONFIG, reduced  # noqa: F401
