from repro.roofline.analysis import (
    PEAKS,
    V5E,
    collective_bytes_from_hlo,
    peaks,
    roofline_report,
)

__all__ = ["PEAKS", "V5E", "collective_bytes_from_hlo", "peaks",
           "roofline_report"]
