"""Traffic kind `serve_open`: see serve.py, which runs both serving kinds."""
from .serve import run  # noqa: F401
