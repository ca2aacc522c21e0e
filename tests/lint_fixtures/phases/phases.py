"""Fixture phase catalog (AST-extracted by the lint, never imported)."""

PHASES = (
    "inputs",
    "advance",
    "checksum",
    "blocked",
    "never_timed",
)
