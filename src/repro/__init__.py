"""Reproduction of spike-coded die-to-die communication, grown toward a
production-scale serving system (see ROADMAP.md)."""
