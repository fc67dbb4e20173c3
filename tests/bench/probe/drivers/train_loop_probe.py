"""A traffic kind that is not built in: the training loop, counting the
units it runs in the window."""
from loops import TrainLoop


class DRIVER(TrainLoop):
    def run(self, on_unit) -> None:
        self.units = 0

        def counted():
            self.units += 1
            on_unit()

        super().run(counted)
