"""The on-chip benchmark's harness: set-up, window, check and metrics."""
