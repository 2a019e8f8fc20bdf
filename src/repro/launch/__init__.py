"""Launchers: the s-step solver's dry-run at the production mesh."""
