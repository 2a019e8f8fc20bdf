"""Launchers: production mesh construction, the multi-pod dry-run, and the
real train/serve drivers."""
