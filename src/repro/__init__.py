"""Slim Fly reproduction framework."""
