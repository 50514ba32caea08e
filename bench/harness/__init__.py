"""The benchmark's yardstick: cell layout, fleet engine driver, reference,
comparison and trace reduction."""
