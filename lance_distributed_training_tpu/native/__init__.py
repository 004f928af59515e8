"""Native (C++) components and their ctypes bindings.

The reference's only native code is upstream pylance's Rust core (SURVEY.md
§2.2); here the native hot path is a libjpeg batch decoder with a C++ thread
pool (:mod:`.jpeg`), built lazily with g++ on first use; a failed build
raises, and ``LDT_DISABLE_NATIVE=1`` selects the pure-Python PIL path.
"""

from .jpeg import (  # noqa: F401
    batch_decode_jpeg,
    batch_decode_jpeg_arrow,
    native_available,
)
