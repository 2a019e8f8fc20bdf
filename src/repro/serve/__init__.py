"""The s-step solve service (`solver_service`) and its slot table (`slots`)."""
