"""Launchers for the hosted jobs (``train``, ``serve``), the persistent
compile cache they share (``cache``) and a device-mesh helper (``mesh``).
Importing the package touches no jax device state."""
